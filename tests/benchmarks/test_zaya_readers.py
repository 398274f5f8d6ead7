"""The ZAYA1-8B serving cell's three metrics on hand-made observations
(what each reads, and that the parent commit's program gives it nothing
to read), and the paged grouped-query decode's cost function at the
cell's shape: 8 query heads on 2 key/value heads of 128, 1,024 B a
position a layer."""

import json
import types
from pathlib import Path

import pytest

from benchmarks.harness import costs, layers, manifest, peaks, readers
from benchmarks.metrics import gqa_decode_cost
from tests.benchmarks.hand_made import program, ran_by

ROOT = Path(__file__).resolve().parents[2]
ZAYA = json.loads(
    (ROOT / "benchmarks/configs/zaya1-8b-decode.json").read_text())
CELL = "zaya1-8b-decode.serve-reason-closed"
SLOTS, CHUNK_K = 256, 8
CCA_MIX = "model.decode_cca_mix_device_pct"
ROUTER = "model.decode_router_device_pct"
SKIPS = "moe.decode_skip_rows_pct"

LAYER = "jit(fused_fn)/while/body/closed_call/M.logits_last/model/layers_3/"
ATTN = LAYER + "self_attn/"
OPS = [
    ("%fusion.1 = bf16[256,1280] fusion(%a)", 30.000, 0.010),
    ("%fusion.2 = f32[256,1280] fusion(%b)", 30.010, 0.020),
    ("%fusion.3 = f32[256,8,128] fusion(%c)", 30.030, 0.005),
    ("%fusion.4 = bf16[256,2,128] fusion(%d)", 30.035, 0.005),
    ("%fusion.5 = bf16[256,8,128] fusion(%e)", 30.040, 0.010),
    ("%fusion.6 = bf16[256,8,128] fusion(%f)", 30.050, 0.010),
    ("%custom-call.7 = bf16[256,8,128] custom-call(%g)", 30.060, 0.040),
    ("%fusion.8 = bf16[256,2048] fusion(%h)", 30.100, 0.020),
    ("%fusion.9 = f32[256,256] fusion(%i)", 30.120, 0.004),
    ("%fusion.10 = f32[256,256] fusion(%j)", 30.124, 0.006),
    ("%fusion.11 = s32[256,1] fusion(%k)", 30.130, 0.010),
    ("%fusion.12 = bf16[256,2048] fusion(%l)", 30.140, 0.060),
]
SCOPES = {
    "fusion.1": ATTN + "cca/qk_proj/q_proj/dot_general",
    "fusion.2": ATTN + "cca/conv/conv0/mul",
    "fusion.3": ATTN + "cca/qk_mean/add",
    "fusion.4": ATTN + "cca/v_shift/v_prev_proj/dot_general",
    "fusion.5": ATTN + "cca/norm_temp/rsqrt",
    "fusion.6": ATTN + "cca/rope/concatenate",
    "custom-call.7":
        ATTN + "self_attn._decode_attend/paged_decode_p8/pallas_call",
    "fusion.8": ATTN + "cca/out_proj/o_proj/dot_general",
    "fusion.9": LAYER + "mlp/router/moe/router/down/down/dot_general",
    "fusion.10": LAYER + "mlp/router/moe/router/mlp/fc1/dot_general",
    "fusion.11": LAYER + "mlp/router/moe/router/select/top_k",
    "fusion.12": LAYER + "mlp/moe/experts/down/all_experts/dot_general",
}
BUSY = 0.200


def a_run(stats=None, **observed):
    o = types.SimpleNamespace(
        stats_window=stats or {}, opened_at=10.0, closed_at=20.0,
        chunk_k=CHUNK_K, slots=SLOTS, **observed)
    return readers.Run(
        cell=types.SimpleNamespace(config=ZAYA), observed=o, setup_s=0.0,
        inventory=(), device_kind="TPU v5 lite")


def test_the_mix_is_what_cca_adds_beside_the_kernel_and_the_out_proj():
    run = ran_by(a_run(), OPS, SCOPES)
    # the six scopes, neither the paged decode call nor cca/out_proj
    assert readers.read(run, CCA_MIX) == pytest.approx(100 * 0.060 / BUSY)
    assert run.notes[CCA_MIX + ".device_s"] == pytest.approx(0.060)
    # the parent's program (a grouped-query layer: no such scope), or no
    # trace: nothing to read
    run.programs = (program({"custom-call.7": ATTN.replace(
        "self_attn/", "self_attn/self_attn._decode_attend/")}),)
    assert readers.read(run, CCA_MIX) is None
    assert readers.read(a_run(), CCA_MIX) is None


def test_the_routers_share_is_its_scopes_over_busy_time():
    run = ran_by(a_run(), OPS, SCOPES)
    assert readers.read(run, ROUTER) == pytest.approx(100 * 0.020 / BUSY)
    assert run.notes[ROUTER + ".device_s"] == pytest.approx(0.020)
    run.programs = (program({"fusion.12": SCOPES["fusion.12"]}),)
    assert readers.read(run, ROUTER) is None
    assert readers.read(a_run(), ROUTER) is None


def test_the_skips_share_is_the_windows_own_counts():
    run = a_run({"moe_rows_held": 9_400, "moe_rows_routed": 10_000,
                 "moe_rows_skipped": 600})
    assert readers.read(run, SKIPS) == 6.0
    assert readers.read(run, "moe.decode_held_rows_pct") == 94.0
    # a router that never skipped reads 0, not nothing; the parent's
    # stats (no such counter) and a model that counts nothing read nothing
    assert readers.read(a_run({
        "moe_rows_held": 5, "moe_rows_routed": 5, "moe_rows_skipped": 0,
    }), SKIPS) == 0.0
    for stats in ({"moe_rows_held": 625, "moe_rows_routed": 10_000},
                  {"moe_rows_routed": 0, "moe_rows_skipped": 0}, {}):
        assert readers.read(a_run(stats), SKIPS) is None


@pytest.mark.parametrize("name,source", [
    (CCA_MIX, "device_trace"), (ROUTER, "device_trace"),
    (SKIPS, "program_counter"),
])
def test_the_metric_is_listed_for_the_cell(name, source):
    entry, = (m for m in manifest.manifest()["per_layer"]
              if m["name"] == name)
    assert CELL in entry["workloads"]
    own = manifest.metric_file(name)
    assert own["reader"] == {"file": True}
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == own[key], key
    assert (own["unit"], own["better"], own["source"], own["layer"],
            own["moves"]) == (
        "%", "lower", source, "model", "serve_tokens_per_s")


def test_a_position_of_the_latent_pool_by_hand():
    """No ``hybrid_layer_pattern``: every layer full, at the plain keys.
    2 heads x (128 keys + 128 values) x 2 B."""
    assert gqa_decode_cost.layer_kinds(ZAYA) == [0] * 12
    assert gqa_decode_cost.kind_sizes(ZAYA, window=False) == {
        "h": 8, "h_kv": 2, "d": 128, "d_v": 128}
    assert gqa_decode_cost.position_bytes(ZAYA, window=False) == 1024
    assert gqa_decode_cost.position_flops(ZAYA, window=False) == 2 * 8 * 256
    # ISSUE 56's pool: 4,609 pages of 64 positions in each of 12 layers
    assert round(12 * 4609 * 64 * 1024 / 1e9, 2) == 3.62
    # the same positions as 8-head uncompressed keys and values
    assert round(12 * 256 * 1152 * 8 * 256 * 2 / 1e9, 1) == 14.5


def test_work_of_a_traced_window_by_hand():
    # 21 chunks of 8 steps, 256 slots, a mean context of 420 positions,
    # no window layer
    slot_steps = 21 * CHUNK_K * SLOTS
    work = gqa_decode_cost.gqa_decode_work(
        ZAYA, positions_attended=slot_steps * 420,
        window_positions_attended=0)
    assert work["bytes"] == slot_steps * 12 * 420 * 1024
    assert work["flops"] == slot_steps * 12 * 420 * 2 * 8 * 256
    least, bound = costs.roofline_seconds(work, peaks.peak_for("TPU v5 lite"))
    # 256 x 5.2 MB a step: memory-bound, 1.6 ms a step
    assert bound == "memory"
    assert least / (21 * CHUNK_K) == pytest.approx(1.61e-3, rel=0.02)


def test_the_roofline_reads_this_cells_kernel_calls(monkeypatch):
    """The paged decode kernel's reader finds the compressed attention's
    calls as it finds MiMo's (the call's own scope), given the counts a
    program without window layers now puts on its spans: 0 for them."""
    slot_steps = CHUNK_K * SLOTS
    meta = {"positions_attended": slot_steps * 420,
            "window_positions_attended": 0, "slot_steps_busy": slot_steps}
    monkeypatch.setattr(layers, "program_spans", lambda: [
        types.SimpleNamespace(
            name="serve/step", t0=30.0, dur_s=0.2, step=9, meta=meta)])
    run = ran_by(a_run(traced=(29.9, 31.0)), OPS, SCOPES)
    least = slot_steps * 12 * 420 * 1024 / 819e9
    assert readers.read(run, "kernel.gqa_decode_roofline") == pytest.approx(
        100 * least / 0.040)
    # the costs the expert products' roofline divides by: every expert
    # held and the router's skip outside the count (no ``share`` block)
    assert costs.routed_per_token(ZAYA) == 1.0
    assert costs.published_experts(ZAYA) == 16
    assert costs.expert_mm_decode(ZAYA, SLOTS, 16.0)["bytes"] == (
        16 * 3 * 2048 * 2048 * 2 + 256 * (2 * 2048 + 4 * 2048) * 2)
