"""The Kimi delta attention decode step's cost function against hand
arithmetic at Solar-Open2-250B's published sizes, and the two readers
that divide by it or take its scope on hand-made observations: what they
read, and that a program without the spans or the scope, or a
configuration of another recurrence, gives them nothing to read."""

import json
import types
from pathlib import Path

import pytest

from benchmarks.harness import costs, layers, peaks, readers
from benchmarks.metrics import kda_decode_cost
from tests.benchmarks.hand_made import program, ran_by

ROOT = Path(__file__).resolve().parents[2]
SOLAR = json.loads((
    ROOT / "benchmarks/configs/solar-open2-250b-share8-decode.json"
).read_text())
GRANITE = json.loads((
    ROOT / "benchmarks/configs/granite-4.0-h-small-share4-decode.json"
).read_text())
SLOTS = SOLAR["serving"]["slots"]
PEAK = peaks.peak_for("TPU v5 lite")


def test_the_state_is_1074_mb_a_layer_and_moves_twice_a_step():
    # 256 rows x 64 heads x 128 x 128 state numbers in float32: 4.19 MB a
    # layer a caller, a Granite Mamba-2 layer's
    assert SLOTS == 256
    assert kda_decode_cost.widths(SOLAR) == (8192, 64, 128, 4)
    assert kda_decode_cost.state_bytes(SOLAR, 1) == 4_194_304
    assert kda_decode_cost.state_bytes(SOLAR, SLOTS) == 1_073_741_824
    # one period of the twelve-long list: GQA at 0
    assert kda_decode_cost.kda_layers(SOLAR) == 3
    assert kda_decode_cost.kda_layers(
        dict(SOLAR, num_hidden_layers=48)) == 36
    resident = 3 * kda_decode_cost.state_bytes(SOLAR, SLOTS)
    assert round(resident / 1e9, 2) == 3.22
    assert round(2 * resident / 1e9, 2) == 6.44  # moved a step
    tails = 3 * SLOTS * 3 * 24_576 * 2
    # what the serve/step spans' recurrent_state_bytes should read
    assert resident + tails == 3_334_471_680
    # the mixers are as wide as the attention heads together, or the file
    # is another model's
    with pytest.raises(AssertionError):
        kda_decode_cost.widths(dict(SOLAR, num_attention_heads=32))


def test_one_layer_one_step_by_hand():
    one = kda_decode_cost.layer_step(SOLAR, SLOTS)
    hd, heads, d, k = 8192, 64, 128, 4
    state = 2 * SLOTS * hd * d * 4
    tail = 2 * SLOTS * (k - 1) * 3 * hd * 2
    # q, k, v in bf16; the decay's and the gate's rows in float32; beta
    operands = SLOTS * (3 * hd * 2 + 2 * hd * 4 + heads * 2)
    assert state == 2_147_483_648
    assert one["bytes"] == state + tail + operands
    assert one["flops"] == SLOTS * (hd * (7 * d + 12) + 3 * hd * 2 * k)
    # memory-bound by two orders: 2.73 ms a layer a step at 819 GB/s
    least, bound = costs.roofline_seconds(one, PEAK)
    assert bound == "memory"
    assert least == pytest.approx(2.733e-3, rel=0.01)


def test_the_projections_ride_along_by_hand():
    """The mixer's 137.7 M parameters in bf16 once a step and two
    operations a matmul weight a row: 280 MB against the state's 2,238,
    and still memory-bound at 256 rows."""
    got = kda_decode_cost.projections(SOLAR, SLOTS)
    e, hd, heads, d, k = 4096, 8192, 64, 128, 4
    matmuls = 3 * e * hd + 2 * (e * d + d * hd) + e * heads + hd * e
    small = 3 * hd * k + heads + 2 * hd + d
    assert matmuls == kda_decode_cost.matmul_params(SOLAR) == 137_625_600
    # tests/nn/test_kda_layer.py's count by abstract shapes
    assert matmuls + small == 137_740_480
    assert got["bytes"] == (matmuls + small) * 2 + 2 * SLOTS * e * 2
    assert got["flops"] == 2 * SLOTS * matmuls
    step = kda_decode_cost.layer_step(SOLAR, SLOTS)
    both = {key: got[key] + step[key] for key in got}
    least, bound = costs.roofline_seconds(both, PEAK)
    assert bound == "memory"
    assert least == pytest.approx(3.092e-3, rel=0.01)


def test_work_scales_with_the_kda_layers_and_the_steps():
    work = kda_decode_cost.kda_decode_work(SOLAR, SLOTS, steps=22 * 8)
    one = kda_decode_cost.layer_step(SOLAR, SLOTS)
    around = kda_decode_cost.projections(SOLAR, SLOTS)
    assert work["bytes"] == (one["bytes"] + around["bytes"]) * 3 * 176
    assert work["flops"] == (one["flops"] + around["flops"]) * 3 * 176
    # 9.3 ms of every decode step, whatever the contexts: 7.60 GB, the
    # 56 % of a step's 13.5 GB the cell's ``why`` states
    least, _ = costs.roofline_seconds(work, PEAK)
    assert least / 176 == pytest.approx(9.275e-3, rel=0.005)
    assert round(work["bytes"] / 176 / 1e9, 2) == 7.60


def test_mamba_2s_count_is_not_borrowed():
    """The two recurrences read different keys: neither file gives the
    other's cost function what it asks for."""
    from benchmarks.metrics import ssm2_decode_cost

    with pytest.raises(KeyError):
        ssm2_decode_cost.ssm2_decode_work(SOLAR, SLOTS, steps=8)
    with pytest.raises(KeyError):
        kda_decode_cost.kda_decode_work(GRANITE, SLOTS, steps=8)


# -- the readers ---------------------------------------------------------------


def span(name, t0, dur_s, step, meta=None):
    return types.SimpleNamespace(
        name=name, t0=t0, dur_s=dur_s, step=step, meta=meta)


def run_of(config=SOLAR, **observed):
    cell = types.SimpleNamespace(config=config)
    o = types.SimpleNamespace(
        stats_window={}, opened_at=10.0, closed_at=20.0, chunk_k=8,
        slots=SLOTS, **observed)
    return readers.Run(cell=cell, observed=o, setup_s=0.0, inventory=(),
                       device_kind="TPU v5 lite")


def with_timeline(monkeypatch, spans):
    monkeypatch.setattr(layers, "program_spans", lambda: list(spans))


STATE = {"recurrent_state_bytes": 3_334_471_680, "rows_reset": 2}
OPS = [
    ("%custom-call.1 = f32[256,64,128,128] custom-call(%a)", 30.00, 0.150),
    ("%fusion.2 = bf16[256,3,24576] fusion(%b)", 30.20, 0.010),
    ("%fusion.3 = bf16[256,8192] fusion(%c)", 30.30, 0.040),
    ("%fusion.4 = bf16[256,4096] fusion(%d)", 30.40, 0.020),
    ("%fusion.5 = bf16[256,1280] fusion(%e)", 30.50, 0.030),
]
LAYER = "jit(f)/while/body/closed_call/M.logits_last/model/layers_1/"
SCOPES = {
    "custom-call.1": LAYER + "kda/kda/state_update/kda_step/pallas_call",
    "fusion.2": LAYER + "kda/kda/conv/concatenate",
    "fusion.3": LAYER + "kda/kda/qkv_proj/k_proj/dot_general",
    "fusion.4": LAYER.replace("_1/", "_0/") + "self_attn/o_proj/dot_general",
    "fusion.5": LAYER + "mlp/moe/experts/down/all_experts/dot_general",
}
ROOFLINE, SHARE = "kernel.kda_decode_roofline", "model.decode_kda_device_pct"


def test_roofline_share_from_the_traced_steps(monkeypatch):
    with_timeline(monkeypatch, [
        span("serve/step", 15.0, 0.2, 3, STATE),   # the window's: left out
        span("serve/step", 30.0, 0.2, 9, STATE),   # inside the capture
        span("serve/step", 30.3, 0.2, 10, STATE),
    ])
    run = ran_by(run_of(traced=(29.9, 31.0)), OPS, SCOPES)
    want = kda_decode_cost.kda_decode_work(SOLAR, SLOTS, steps=16)
    least, _ = costs.roofline_seconds(want, run.peak)
    got = readers.read(run, ROOFLINE)
    # every op under a mixer's scope, its projections too (the state's
    # traffic hides under them), and not the attention layer's or the
    # experts'
    assert got == pytest.approx(100.0 * least / 0.200)
    assert run.notes[ROOFLINE + ".bound"] == "memory"
    assert run.notes[ROOFLINE + ".traced_chunks"] == 2
    assert run.notes[ROOFLINE + ".device_s"] == pytest.approx(0.200)
    # no capture, no op under the scope, spans without the count, or a
    # configuration of another recurrence (Mamba-2's keys): nothing
    assert readers.read(run_of(traced=None), ROOFLINE) is None
    run.programs = (program({"fusion.4": SCOPES["fusion.4"]}),)
    assert readers.read(run, ROOFLINE) is None
    run.programs = (program(SCOPES),)
    with_timeline(monkeypatch, [span("serve/step", 30.0, 0.2, 9)])
    assert readers.read(run, ROOFLINE) is None
    other = ran_by(run_of(config=GRANITE, traced=(29.9, 31.0)), OPS, SCOPES)
    assert readers.read(other, ROOFLINE) is None


def test_kda_share_is_the_mixers_scope_over_busy_time():
    run = ran_by(run_of(), OPS, SCOPES)
    assert readers.read(run, SHARE) == pytest.approx(100.0 * 0.200 / 0.250)
    assert run.notes[SHARE + ".device_s"] == pytest.approx(0.200)
    # a program with no op under a mixer's scope, or no trace: nothing
    run.programs = (program({"fusion.4": SCOPES["fusion.4"]}),)
    assert readers.read(run, SHARE) is None
    assert readers.read(run_of(), SHARE) is None


@pytest.mark.parametrize("name,layer", [(ROOFLINE, "kernels"),
                                        (SHARE, "model")])
def test_the_metric_is_listed_for_its_cell_alone(name, layer):
    from benchmarks.harness import manifest

    entry, = (m for m in manifest.manifest()["per_layer"]
              if m["name"] == name)
    assert entry["workloads"] == [
        "solar-open2-250b-share8-decode.serve-reason-closed"]
    own = manifest.metric_file(name)
    assert own["reader"] == {"file": True}
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == own[key], key
    assert (own["unit"], own["better"], own["source"], own["layer"],
            own["moves"]) == (
        "%", "higher", "device_trace", layer, "serve_tokens_per_s")
