"""The n-stream residual path's cost function against hand arithmetic at
Xing4.0-29B-A4B's published sizes, and this PR's four readers on
hand-made observations: what they read, and that a program without the
scopes or counters gives them nothing to read (the parent commit under
these files).

The arithmetic (ISSUE 35): with n = 4 streams of C = 3,584, one sublayer
and one token move, forward, the stream read twice (to form coefficients
and the sublayer's input; to mix the residual) and written once, the
C-wide input written and the output read: (3 n + 2) C = 50,176 bf16
numbers = 100,352 B; the backward twice that. 8,192 tokens: 2.47 GB a
sublayer a step. 12 sublayers (5 layers and the MTP block, two each):
29.6 GB a step, 36 ms at 819 GB/s."""

import json
import types
from pathlib import Path

import pytest

from benchmarks.harness import costs, peaks, readers
from benchmarks.metrics import mhc_train_cost
from tests.benchmarks.hand_made import STEP, program, ran_by
from tests.conftest import load_repo_module

in_order = load_repo_module(
    "bench_run_tiny", "tests/benchmarks/test_run_tiny.py").in_order
ROOT = Path(__file__).resolve().parents[2]
XING = json.loads(
    (ROOT / "benchmarks/configs/xing4.0-29b-a4b-share8.json").read_text()
)
CELL = "xing4.0-29b-a4b-share8.train-8k"
TOKENS = 2 * 4096
PEAK = peaks.peak_for("TPU v5 lite")


def test_one_sublayer_one_token_by_hand():
    one = mhc_train_cost.sublayer_token_forward(XING)
    assert mhc_train_cost.streams(XING) == 4
    assert one["bytes"] == (3 * 4 + 2) * 3584 * 2 == 100_352
    # the 24-column coefficient matmul, the input mix, the 4 x 5 update
    assert one["flops"] == 2 * 14336 * 24 + 2 * 14336 + 2 * 4 * 5 * 3584


def test_a_step_moves_29_6_gb_in_36_ms():
    assert mhc_train_cost.sublayers(XING) == 12
    work = mhc_train_cost.mhc_train_work(XING, TOKENS)
    a_sublayer = 3 * TOKENS * 100_352
    assert round(a_sublayer / 1e9, 2) == 2.47
    assert work["bytes"] == 12 * a_sublayer
    assert round(work["bytes"] / 1e9, 1) == 29.6
    least, bound = costs.roofline_seconds(work, PEAK)
    assert bound == "memory"  # 20 GFLOP against 29.6 GB: by two orders
    assert least == pytest.approx(36.1e-3, rel=0.01)


@pytest.mark.parametrize("changes,sublayers,streams", [
    ({}, 12, 4),
    ({"num_nextn_predict_layers": 0}, 10, 4),
    ({"num_hidden_layers": 40}, 82, 4),
    ({"hc_mult": 2}, 12, 2),
], ids=["the_cell", "no_module", "full_depth", "two_streams"])
def test_work_scales_with_the_blocks_and_the_streams(changes, sublayers, streams):
    cfg = dict(XING, **changes)
    assert mhc_train_cost.sublayers(cfg) == sublayers
    work = mhc_train_cost.mhc_train_work(cfg, TOKENS)
    assert work["bytes"] == (
        3 * TOKENS * sublayers * (3 * streams + 2) * 3584 * 2
    )


def test_a_configuration_without_the_path_has_one_stream():
    deepseek = json.loads(
        (ROOT / "benchmarks/configs/deepseek-v2-lite-l2.json").read_text())
    assert mhc_train_cost.streams(deepseek) == 1


def test_the_share_cut_reads_these_costs():
    """What ``test_costs.py`` pins for the configurations of PR 33, for
    this one (that test is parametrised over those six alone): every
    digit, no tolerance, at the cell's 2 x 4,096 tokens."""
    assert "share" in XING
    observed = types.SimpleNamespace(
        tokens_per_step=TOKENS, seq_len=4096, chips=1)
    run = types.SimpleNamespace(hf=XING, observed=observed)
    assert costs.n_dense_layers(XING) == 1
    assert costs.n_routed_experts(XING) == 8
    assert costs.published_experts(XING) == 64
    assert costs.routed_per_token(XING) == 0.5
    assert costs.attention_matmul_params(XING) == 28_409_856
    assert costs.active_matmul_params(XING) == 496_402_432.0
    assert costs.attention_score_flops_per_token(XING, 4096) == 251_658_240
    assert costs.train_flops_per_token(XING, 4096) == 3_733_389_312.0
    assert costs.expert_mm_train(XING, TOKENS) == {
        "flops": 270_582_939_648.0, "bytes": 805_306_368.0}
    assert costs.flash_train(XING, 2, 4096) == {
        "flops": 1_202_590_842_880.0, "bytes": 1_006_632_960.0}
    # five expert blocks and six attention blocks: the module's are trained
    assert readers._expert_mm_train(run) == {
        "flops": 1_352_914_698_240.0, "bytes": 4_026_531_840.0}
    assert readers._flash_train(run) == {
        "flops": 7_215_545_057_280.0, "bytes": 6_039_797_760.0}
    # a step is 30.58 TFLOP: 0.155 s at the chip's peak
    assert round(TOKENS * 3_733_389_312.0 / 1e12, 2) == 30.58


# -- the readers ---------------------------------------------------------------


def span(name, t0, dur_s, step, meta=None):
    return types.SimpleNamespace(
        name=name, t0=t0, dur_s=dur_s, step=step, meta=meta)


def run_of(config=XING, **observed):
    cell = types.SimpleNamespace(config=config)
    o = types.SimpleNamespace(
        opened_at=10.0, closed_at=20.0, tokens_per_step=TOKENS, chips=1,
        seq_len=4096, **observed,
    )
    return readers.Run(cell=cell, observed=o, setup_s=0.0, inventory=(),
                       device_kind="TPU v5 lite")


def with_timeline(monkeypatch, spans):
    from benchmarks.harness import layers

    monkeypatch.setattr(layers, "program_spans", lambda: list(spans))


def traced(run, ops=None, steps=2):
    """``run`` as ``steps`` executions of the step program ran ``OPS``."""
    return ran_by(run, ops or OPS, SCOPES, STEP, executions=steps)


OPS = [
    ("%fusion.1 = bf16[2,4096,4,3584] fusion(%a)", 30.00, 0.030),
    ("%fusion.2 = f32[24,2,4096] fusion(%b)", 30.04, 0.010),
    ("%fusion.3 = bf16[2,4096,3584] fusion(%c)", 30.06, 0.020),
    ("%fusion.4 = bf16[8192,3584] fusion(%d)", 30.09, 0.040),
    ("%fusion.5 = bf16[2,4096,4,3584] fusion(%e)", 30.14, 0.008),
]
JIT = "jit(step)/jit(main)/transpose(jvp(DeepseekCausalLM))/"
SCOPES = {
    "fusion.1": JIT + "model/layers_1/attn_mhc/mhc/post/mul",
    "fusion.2": JIT + "model/layers_1/mlp_mhc/mhc/sinkhorn/div",
    "fusion.3": JIT + "mtp/block/mlp_mhc/mhc/pre/mul",
    "fusion.4": JIT + "mtp/block/self_attn/o_proj/dot_general",
    "fusion.5": JIT + "model/mhc/expand/broadcast_in_dim",
}
BUSY = sum(op[2] for op in OPS)


def test_residual_mix_share_is_the_mhc_scopes_over_busy_time():
    run = traced(run_of())
    assert readers.read(run, "model.train_residual_mix_device_pct") == \
        pytest.approx(100.0 * 0.068 / BUSY)
    # a program without the path, or no trace: nothing
    run.programs = (program({"fusion.4": SCOPES["fusion.4"]}, STEP),)
    assert readers.read(run, "model.train_residual_mix_device_pct") is None
    assert readers.read(run_of(), "model.train_residual_mix_device_pct") is None


def test_mtp_share_is_the_modules_scope_and_overlaps_the_others():
    run = traced(run_of())
    # the module's stream mix and its attention projection, both
    assert readers.read(run, "model.train_mtp_device_pct") == \
        pytest.approx(100.0 * 0.060 / BUSY)
    run.programs = (program({"fusion.1": SCOPES["fusion.1"]}, STEP),)
    assert readers.read(run, "model.train_mtp_device_pct") is None
    assert readers.read(run_of(), "model.train_mtp_device_pct") is None


def test_roofline_share_from_the_traced_steps():
    run = traced(run_of(), steps=2)
    least, _ = costs.roofline_seconds(
        mhc_train_cost.mhc_train_work(XING, TOKENS), PEAK)
    got = readers.read(run, "kernel.mhc_train_roofline")
    assert got == pytest.approx(100.0 * 2 * least / 0.068)
    assert run.notes["kernel.mhc_train_roofline.bound"] == "memory"
    assert run.notes["kernel.mhc_train_roofline.traced_steps"] == 2
    # no trace, no op under the scope, no step program, or a
    # configuration with one stream: nothing
    assert readers.read(run_of(), "kernel.mhc_train_roofline") is None
    run.programs = (program({"fusion.4": SCOPES["fusion.4"]}, STEP),)
    assert readers.read(run, "kernel.mhc_train_roofline") is None
    assert readers.read(
        traced(run, steps=0), "kernel.mhc_train_roofline") is None
    plain = traced(run_of(config=dict(XING, hc_mult=1)))
    assert readers.read(plain, "kernel.mhc_train_roofline") is None


def test_held_rows_share_is_the_windows_counts(monkeypatch):
    counts = {"moe/rows_held": 20_000.0, "moe/rows_routed": 163_840.0}
    with_timeline(monkeypatch, [
        span("train/step", 5.0, 0.4, 2, {"moe/rows_held": 1.0,
                                         "moe/rows_routed": 1.0}),  # warm-up
        span("train/step", 11.0, 0.4, 10, counts),
        span("train/step", 12.0, 0.4, 11),  # a step that fetched nothing
        span("train/step", 15.0, 0.4, 20, dict(counts, **{
            "moe/rows_held": 21_000.0})),
        span("train/phase/metric_flush", 15.3, 0.1, 20),
    ])
    assert readers.read(run_of(), "moe.held_rows_pct") == \
        pytest.approx(100.0 * 41_000 / 327_680)
    # the parent's spans carry no such counts; nor does a program whose
    # layers hold every expert
    with_timeline(monkeypatch, [
        span("train/step", 11.0, 0.4, 10),
        span("train/step", 12.0, 0.4, 11, {"moe/ep_buffer_fill": 0.8}),
    ])
    assert readers.read(run_of(), "moe.held_rows_pct") is None


# what every one-chip training cell reports, in the manifest's order
EVERY_TRAINING_CELL = [
    "entry.compile_s", "entry.train_compiles_in_window", "train.mfu_pct",
    "train.host_unthrottled_step_pct", "step.train_step_device_ms",
    "step.hbm_claim_gb", "kernel.expert_mm_train_roofline",
    "kernel.flash_train_roofline", "device.train_idle_pct", "entry.lower_s",
    "model.train_experts_device_pct", "model.train_attention_device_pct",
    "model.train_head_loss_device_pct", "model.train_optimizer_device_pct",
]
OWN = ["model.train_residual_mix_device_pct", "kernel.mhc_train_roofline",
       "model.train_mtp_device_pct", "moe.held_rows_pct"]


def check_the_manifest_gives_the_cell_its_metrics(root=ROOT):
    """Held to what the test states as a subsequence, never to the whole
    list: a later PR appends an entry with files and entries alone."""
    from benchmarks.harness import manifest

    cell = manifest.cell(CELL, root=root)
    deepseek = manifest.cell("deepseek-v2-lite-l2.train-16k", root=root)
    names = [m["name"] for m in cell.per_layer]
    # every training metric the one-chip cells report, and four of its own
    assert in_order(EVERY_TRAINING_CELL + OWN, names)
    assert in_order(
        EVERY_TRAINING_CELL, [m["name"] for m in deepseek.per_layer])
    assert not set(OWN) & {m["name"] for m in deepseek.per_layer}
    assert "shard.collective_exposed_pct" not in names
    assert [m["name"] for m in cell.end_to_end] == \
        [m["name"] for m in deepseek.end_to_end]
    assert cell.chips == 1 and cell.traffic["kind"] == "train_steps"
    assert (cell.traffic["sequences"], cell.traffic["seq_len"]) == (2, 4096)
    assert cell.config["share"] == {
        "published": {"n_routed_experts": 64, "vocab_size": 131072}}
    # the share is what the cost functions read: an eighth of the rows
    assert costs.routed_per_token(cell.config) == 0.5
    assert costs.n_trained_sparse_layers(cell.config) == 5
    assert costs.n_trained_attention_layers(cell.config) == 6


def test_the_manifest_gives_the_cell_its_metrics():
    check_the_manifest_gives_the_cell_its_metrics()
