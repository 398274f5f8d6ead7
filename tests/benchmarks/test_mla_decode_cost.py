"""The absorbed latent decode's cost function against hand arithmetic at
GLM-4.7-Flash's published sizes, and this PR's three readers on hand-made
observations: what they read, and that a program without the counters
gives them nothing to read (the parent commit under these files)."""

import json
import types
from pathlib import Path

import pytest

from benchmarks.harness import costs, peaks, readers
from benchmarks.metrics import mla_decode_cost
from tests.benchmarks.hand_made import program, ran_by

ROOT = Path(__file__).resolve().parents[2]
GLM = json.loads(
    (ROOT / "benchmarks/configs/glm-4.7-flash-decode.json").read_text()
)


def test_a_position_of_the_latent_pool_is_1152_bytes():
    # 512 latent + 64 rotary-key numbers in bf16; Qwen3's GQA pool holds
    # 2 (k, v) x 4 heads x 128 x 2 B = 2,048 B a position a layer
    assert mla_decode_cost.position_bytes(GLM) == 1152
    pool = 64 * 1152 * GLM["num_hidden_layers"] * 1152
    assert round(pool / 1e9, 2) == 0.51


def test_work_of_one_row_one_step_by_hand():
    one = mla_decode_cost.mla_decode_work(
        GLM, positions_attended=1, slot_steps=1, steps=1
    )
    h, r, layers = 20, 512, 6
    absorb_and_fold = 2 * h * 192 * r + 2 * h * r * 256
    attend = 2 * h * (r + 64) + 2 * h * r  # scores, then the weighted sum
    assert one["flops"] == layers * (absorb_and_fold + attend)
    weights = r * h * (192 + 256) * 2
    assert one["bytes"] == layers * (1152 + weights)


def test_work_is_linear_in_what_was_attended():
    # a traced window of 16 chunks of 8 steps, 64 slots at the table's
    # mean context of 352 positions
    steps, slots = 16 * 8, 64
    work = mla_decode_cost.mla_decode_work(
        GLM, positions_attended=steps * slots * 352,
        slot_steps=steps * slots, steps=steps,
    )
    rows = steps * slots * 352 * 1152 * 6
    weights = steps * 512 * 20 * 448 * 2 * 6
    assert work["bytes"] == rows + weights
    least, bound = costs.roofline_seconds(work, peaks.peak_for("TPU v5 lite"))
    # 64 x 352 x 1,152 B = 26 MB a layer a step: memory-bound, 0.26 ms a step
    assert bound == "memory"
    assert least / steps == pytest.approx(0.26e-3, rel=0.05)


# -- the readers ---------------------------------------------------------------


def span(name, t0, dur_s, step, meta=None):
    return types.SimpleNamespace(
        name=name, t0=t0, dur_s=dur_s, step=step, meta=meta
    )


def run_of(stats_window, **observed):
    cell = types.SimpleNamespace(config=GLM)
    o = types.SimpleNamespace(
        stats_window=stats_window, opened_at=10.0, closed_at=20.0,
        chunk_k=8, **observed,
    )
    return readers.Run(cell=cell, observed=o, setup_s=0.0, inventory=(),
                       device_kind="TPU v5 lite")


def with_timeline(monkeypatch, spans):
    from benchmarks.harness import layers

    monkeypatch.setattr(layers, "program_spans", lambda: list(spans))


def test_mean_context_is_positions_over_busy_slot_steps():
    run = run_of({"slot_steps_busy": 1000, "positions_attended": 352_000})
    assert readers.read(run, "serve.mean_context_tokens") == 352.0
    # the parent's ServeStats has no such counter: nothing to read
    assert readers.read(
        run_of({"slot_steps_busy": 1000}), "serve.mean_context_tokens"
    ) is None
    assert readers.read(
        run_of({"slot_steps_busy": 0, "positions_attended": 0}),
        "serve.mean_context_tokens",
    ) is None


def test_pool_use_is_the_windows_peak_over_the_pool(monkeypatch):
    counts = [(700, 452), (810, 342), (760, 392)]
    spans = [span("serve/step", 5.0, 0.2, 0,
                  {"pool_pages": 1100, "pool_pages_free": 52})]  # pre-roll
    spans += [
        span("serve/step", 11.0 + i, 0.2, i + 1,
             {"pool_pages": used, "pool_pages_free": free})
        for i, (used, free) in enumerate(counts)
    ]
    spans.append(span("serve/phase/admit", 12.0, 0.01, 2))
    with_timeline(monkeypatch, spans)
    run = run_of({})
    assert readers.read(run, "serve.latent_pool_used_pct") == \
        pytest.approx(100.0 * 810 / 1152)
    # the parent's serve/step spans carry no counts
    with_timeline(monkeypatch, [span("serve/step", 11.0, 0.2, 1)])
    assert readers.read(run, "serve.latent_pool_used_pct") is None


def test_roofline_share_from_the_traced_chunks_own_counts(monkeypatch):
    from benchmarks.harness import trace as tr

    ops = [
        ("%fusion.1 = f32[64,20,1,1152] fusion(%a)", 30.00, 0.004),
        ("%fusion.2 = f32[64,1,20,512] fusion(%b)", 30.01, 0.002),
        ("%fusion.3 = bf16[64,2048] fusion(%c)", 30.02, 0.010),  # elsewhere
        ("%fusion.4 = bf16[1152,64,512] fusion(%d)", 30.04, 0.003),
    ]
    scopes = {
        "fusion.1": "jit(f)/while/body/layers_1/self_attn/mla/latent_attend/dot",
        "fusion.2": "jit(f)/while/body/layers_1/self_attn/mla/absorb_q/dot",
        "fusion.3": "jit(f)/while/body/layers_1/self_attn/mla/q_up/dot",
        "fusion.4": "jit(f)/while/body/layers_1/self_attn/mla/cache_append/gather",
    }
    chunk = {"positions_attended": 8 * 64 * 352, "slot_steps_busy": 8 * 64,
             "pool_pages": 700, "pool_pages_free": 452}
    with_timeline(monkeypatch, [
        span("serve/step", 15.0, 0.2, 3, chunk),   # the window's: left out
        span("serve/step", 30.0, 0.2, 9, chunk),   # inside the capture
        span("serve/step", 30.3, 0.2, 10, chunk),
    ])
    run = ran_by(run_of({}, traced=(29.9, 31.0)), ops, scopes)
    want = mla_decode_cost.mla_decode_work(
        GLM, positions_attended=2 * 8 * 64 * 352, slot_steps=2 * 8 * 64,
        steps=16,
    )
    least, _ = costs.roofline_seconds(want, run.peak)
    got = readers.read(run, "kernel.mla_decode_roofline")
    assert got == pytest.approx(100.0 * least / 0.009)
    assert run.notes["kernel.mla_decode_roofline.bound"] == "memory"
    assert run.notes["kernel.mla_decode_roofline.traced_chunks"] == 2
    assert tr.parse_op(ops[0][0])[0] == "fusion.1"
    # no capture, no counts on the spans, or no op under the scopes: nothing
    plain = run_of({}, traced=None)
    assert readers.read(plain, "kernel.mla_decode_roofline") is None
    run.programs = (program({}),)
    assert readers.read(run, "kernel.mla_decode_roofline") is None
    with_timeline(monkeypatch, [span("serve/step", 30.0, 0.2, 9)])
    run.programs = (program(scopes),)
    assert readers.read(run, "kernel.mla_decode_roofline") is None
