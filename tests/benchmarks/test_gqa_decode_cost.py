"""The paged grouped-query decode's cost function against hand arithmetic
at MiMo-V2-Flash's published sizes, the serving costs the harness reads
of this share-cut configuration pinned to the last digit (``test_costs.py``
pins the six configurations of PR 33 alone), and this PR's readers on
hand-made observations: what they read, and that a program without the
counters gives them nothing to read (the parent commit under these
files)."""

import json
import types
from pathlib import Path

import pytest

from benchmarks.harness import costs, peaks, readers
from benchmarks.metrics import gqa_decode_cost

ROOT = Path(__file__).resolve().parents[2]
MIMO = json.loads((
    ROOT / "benchmarks/configs/mimo-v2-flash-share16-decode.json"
).read_text())
QWEN = json.loads(
    (ROOT / "benchmarks/configs/qwen3-30b-a3b-decode.json").read_text()
)
CELL = "mimo-v2-flash-share16-decode.serve-reason-closed"
SLOTS, CHUNK_K = 256, 8


def test_a_position_of_each_kind_by_hand():
    # 192 key + 128 value numbers a head in bf16: 4 heads full, 8 window
    assert gqa_decode_cost.kind_sizes(MIMO, window=False) == {
        "h": 64, "h_kv": 4, "d": 192, "d_v": 128}
    assert gqa_decode_cost.kind_sizes(MIMO, window=True) == {
        "h": 64, "h_kv": 8, "d": 192, "d_v": 128}
    assert gqa_decode_cost.position_bytes(MIMO, window=False) == 2560
    assert gqa_decode_cost.position_bytes(MIMO, window=True) == 5120
    assert gqa_decode_cost.position_flops(MIMO, window=True) == 2 * 64 * 320
    # the run's seven layers of the published 48-long pattern
    assert gqa_decode_cost.layer_kinds(MIMO) == [0, 1, 1, 1, 1, 0, 1]
    # a stack of one kind: every layer full, at the plain keys
    assert gqa_decode_cost.layer_kinds(QWEN) == [0] * 6
    assert gqa_decode_cost.position_bytes(QWEN, window=False) == 2048


def test_the_caches_the_issue_reckoned():
    """ISSUE 41's bytes at 256 slots x 1,152 positions: what the
    algorithm holds a position (the chip stores a 192-wide key row as
    256: PERF.md has the chip's own)."""
    full = 2 * 256 * 1152 * gqa_decode_cost.position_bytes(MIMO, False)
    ring = 5 * 256 * 192 * gqa_decode_cost.position_bytes(MIMO, True)
    whole = 5 * 256 * 1152 * gqa_decode_cost.position_bytes(MIMO, True)
    assert (round(full / 1e9, 2), round(ring / 1e9, 2)) == (1.51, 1.26)
    assert round(whole / 1e9, 2) == 7.55  # a context's pages: would not fit


def test_work_of_a_traced_window_by_hand():
    # 21 chunks of 8 steps, 256 slots, the table's mean context of 352
    # positions, every context past the window of 128
    slot_steps = 21 * CHUNK_K * SLOTS
    work = gqa_decode_cost.gqa_decode_work(
        MIMO, positions_attended=slot_steps * 352,
        window_positions_attended=5 * slot_steps * 128,
    )
    rows = slot_steps * (2 * 352 * 2560 + 5 * 128 * 5120)
    assert work["bytes"] == rows
    assert work["flops"] == slot_steps * 2 * 64 * 320 * (2 * 352 + 5 * 128)
    least, bound = costs.roofline_seconds(work, peaks.peak_for("TPU v5 lite"))
    # 256 x (1.8 + 3.3) MB a step: memory-bound, 1.6 ms a step
    assert bound == "memory"
    assert least / (21 * CHUNK_K) == pytest.approx(1.59e-3, rel=0.02)


def test_the_share_cut_reads_these_serving_costs():
    """Every digit, no tolerance, at the cell's 256 slots and chunk of 8:
    what ``kernel.expert_mm_decode_roofline`` divides by."""
    assert "share" in MIMO
    assert costs.n_dense_layers(MIMO) == 1 and costs.n_sparse_layers(MIMO) == 6
    assert costs.n_routed_experts(MIMO) == 16
    assert costs.published_experts(MIMO) == 256
    assert costs.routed_per_token(MIMO) == 0.5
    touched = costs.expected_experts_touched(MIMO, SLOTS)
    assert touched == 15.995275899215436
    assert costs.expert_mm_decode(MIMO, SLOTS, touched) == {
        "flops": 6_442_450_944.0, "bytes": 809_262_900.2221948}
    run = types.SimpleNamespace(
        hf=MIMO, observed=types.SimpleNamespace(slots=SLOTS, chunk_k=CHUNK_K))
    assert readers._expert_mm_decode(run) == {
        "flops": 309_237_645_312.0, "bytes": 38_844_619_210.66535}
    # 4.85 GB of held experts a step: 5.9 ms at the chip's 819 GB/s
    assert round(38_844_619_210 / CHUNK_K / 1e9, 2) == 4.86


# -- the readers ---------------------------------------------------------------


def span(meta):
    return types.SimpleNamespace(
        name="serve/step", t0=1.0, dur_s=0.1, step=0, meta=meta)


def run_with(monkeypatch, spans, stats=None):
    from benchmarks.harness import layers

    monkeypatch.setattr(layers, "program_spans", lambda: spans)
    observed = types.SimpleNamespace(
        opened_at=0.0, closed_at=10.0, stats_window=stats or {},
        traced=None, slots=SLOTS, chunk_k=CHUNK_K,
    )
    return readers.Run(
        cell=types.SimpleNamespace(config=MIMO), observed=observed,
        setup_s=0.0, inventory=(), device_kind="TPU v5 lite",
    )


def test_window_cache_gb_is_the_spans_own_level(monkeypatch):
    ring = 5 * 256 * 192 * 8 * (256 + 128) * 2  # keys stored 256 wide
    run = run_with(monkeypatch, [
        span({"window_cache_bytes": ring}), span({"window_cache_bytes": ring}),
        span(None),
    ])
    assert readers.read(run, "serve.window_cache_gb") == ring / 1e9
    assert round(ring / 1e9, 2) == 1.51
    # the parent's spans carry no such count
    run = run_with(monkeypatch, [span({"recurrent_state_bytes": 0})])
    assert readers.read(run, "serve.window_cache_gb") is None


def test_held_rows_share_is_the_windows_own_counts(monkeypatch):
    run = run_with(monkeypatch, [], stats={
        "moe_rows_held": 625, "moe_rows_routed": 10_000})
    assert readers.read(run, "moe.decode_held_rows_pct") == 6.25
    # a program that counts nothing, and the parent's stats
    for stats in ({"moe_rows_held": 0, "moe_rows_routed": 0}, {}):
        run = run_with(monkeypatch, [], stats=stats)
        assert readers.read(run, "moe.decode_held_rows_pct") is None


@pytest.mark.parametrize("name", [
    "kernel.gqa_decode_roofline", "model.decode_window_attention_device_pct",
])
def test_the_trace_readers_read_nothing_without_a_trace(monkeypatch, name):
    run = run_with(monkeypatch, [span({"window_positions_attended": 1,
                                       "positions_attended": 1})])
    assert readers.read(run, name) is None


def hlo(scope_of: dict) -> str:
    lines = "\n".join(
        f'  %{name} = bf16[256,64,128]{{2,1,0}} custom-call(%p0), '
        f'custom_call_target="tpu_custom_call", '
        f'metadata={{op_name="{scope}"}}'
        for name, scope in scope_of.items()
    )
    return (
        "HloModule jit_fused_fn\n\n"
        "ENTRY %main (p0: bf16[8]) -> bf16[8] {\n"
        "  %p0 = bf16[8]{0} parameter(0)\n" + lines + "\n}\n"
    )


def traced_run(monkeypatch, scope_of, seconds, chunk_meta):
    """A hand-made trace of one execution of the fused chunk: one event an
    instruction, end to end, under the module ``jit_fused_fn``."""
    from benchmarks.harness import layers

    result = "bf16[256,64,128]{2,1,0}"
    ops, t = [], 0.0
    for name, dur in seconds.items():
        ops.append((f"%{name} = {result} custom-call(%p0)", t, dur))
        t += dur
    trace = {
        "devices": {"/device:TPU:0": {
            "ops": ops, "modules": [("jit_fused_fn(123)", 0.0, t)],
        }},
        "host": [],
    }
    run = run_with(monkeypatch, [span(chunk_meta)])
    run.observed.traced = (0.0, 10.0)
    run.trace = trace
    run.programs = (layers.compiled_program(hlo(scope_of)),)
    return run


SCOPES = {
    "custom-call.1": "jit(fused_fn)/layers_0/self_attn/paged_decode_p8/pallas_call",
    "custom-call.2":
        "jit(fused_fn)/layers_1/attn_window/self_attn/paged_decode_p3/pallas_call",
    "custom-call.3": "jit(fused_fn)/layers_1/attn_window/self_attn/o_proj/dot",
    "custom-call.4": "jit(fused_fn)/layers_1/mlp/moe/experts/down",
}


def test_the_roofline_takes_the_kernels_calls_and_the_chunks_counts(
        monkeypatch):
    slot_steps = CHUNK_K * SLOTS
    meta = {"positions_attended": slot_steps * 352,
            "window_positions_attended": 5 * slot_steps * 128,
            "slot_steps_busy": slot_steps}
    seconds = {"custom-call.1": 4e-3, "custom-call.2": 12e-3,
               "custom-call.3": 1e-3, "custom-call.4": 40e-3}
    run = traced_run(monkeypatch, SCOPES, seconds, meta)
    work = gqa_decode_cost.gqa_decode_work(
        MIMO, meta["positions_attended"], meta["window_positions_attended"])
    least = work["bytes"] / 819e9
    got = readers.read(run, "kernel.gqa_decode_roofline")
    # both kernels' calls and nothing else under the attention scopes
    assert got == pytest.approx(100 * least / 16e-3)
    assert 0 < got < 100
    assert run.notes["kernel.gqa_decode_roofline.bound"] == "memory"
    assert run.notes["kernel.gqa_decode_roofline.traced_chunks"] == 1
    # spans without the window layers' count: the parent's program
    bare = traced_run(monkeypatch, SCOPES, seconds,
                      {"positions_attended": 1, "slot_steps_busy": 1})
    assert readers.read(bare, "kernel.gqa_decode_roofline") is None


def test_the_window_layers_share_is_their_scopes_self_time(monkeypatch):
    seconds = {"custom-call.1": 4e-3, "custom-call.2": 12e-3,
               "custom-call.3": 1e-3, "custom-call.4": 40e-3}
    run = traced_run(monkeypatch, SCOPES, seconds, {})
    got = readers.read(run, "model.decode_window_attention_device_pct")
    assert got == pytest.approx(100 * 13e-3 / 57e-3)
    # a program with no such scope
    plain = {k: v.replace("attn_window/", "") for k, v in SCOPES.items()}
    run = traced_run(monkeypatch, plain, seconds, {})
    assert readers.read(run, "model.decode_window_attention_device_pct") is None
