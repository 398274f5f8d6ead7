"""The per-kind training costs (``benchmarks/metrics/
attention_kinds_train_cost.py``) against hand arithmetic at Laguna-XS.2's
published sizes, against ``benchmarks/harness/costs.py`` on the files
whose layers are of one kind (equal to the digit, so that a ``benchmark``
PR can fold one into the other), and this PR's five readers on hand-made
observations: what they read, and that a program without the scopes or
counters gives them nothing to read (the parent commit under these
files).

The arithmetic (ISSUE 44): d = 2,048, heads of 128 on 8 key/value heads;
a full layer's projections with its 48 gate logits 29,458,432, a sliding
layer's with 64 query heads 37,879,808; a token multiplies 275.8 M weights
(attention 172.6 M, the dense layer 50.3 M, four expert layers' router,
one held expert of its eight and the shared one 27.3 M, the head 25.7 M);
scores at 4,096: 2,048 keys a query in a full layer, 480.06 under a window
of 512; 699.5 M FLOPs a token forward, where ``costs.py`` counts 751.8 M
at 48 heads and the causal half in every layer."""

import json
import types
from pathlib import Path

import pytest

from benchmarks.harness import costs, layers, peaks, readers
from benchmarks.metrics import attention_kinds_train_cost as by_kind

ROOT = Path(__file__).resolve().parents[2]


def config(name: str) -> dict:
    return json.loads(
        (ROOT / "benchmarks/configs" / f"{name}.json").read_text())


LAGUNA = config("laguna-xs.2-share8")
CELL = "laguna-xs.2-share8.train-16k-sample4k"
SEQUENCES, SEQ = 4, 4096
TOKENS = SEQUENCES * SEQ
PEAK = peaks.peak_for("TPU v5 lite")
OWN = [
    "train.mfu_by_kind_pct", "kernel.flash_window_train_roofline",
    "kernel.flash_full_train_roofline",
    "model.train_window_attention_device_pct",
    "kernel.flash_window_blocks_computed_pct",
]


def test_the_layers_are_read_from_the_files_own_lists():
    full = by_kind.Layer(by_kind.FULL, 48, None)
    sliding = by_kind.Layer(by_kind.SLIDING, 64, 512)
    assert by_kind.trained_layers(LAGUNA) == [
        full, sliding, sliding, sliding, full]
    assert by_kind.attention_matmul_params(LAGUNA, full) == 29_458_432
    assert by_kind.attention_matmul_params(LAGUNA, sliding) == 37_879_808
    assert by_kind.shared_experts(LAGUNA) == 1.0
    assert by_kind.keys_per_query(SEQ, None) == 2048
    assert by_kind.keys_per_query(SEQ, 512) == 512 - 512 * 511 / 8192
    assert round(by_kind.keys_per_query(SEQ, 512), 1) == 480.1
    assert by_kind.keys_per_query(256, 512) == 128  # a window past the end


def test_a_token_by_hand_at_the_published_sizes():
    attention = 2 * 29_458_432 + 3 * 37_879_808
    expert = 3 * 2048 * 512
    sparse = 2048 * 256 + (8 * 32 / 256 + 1) * expert
    head = 2048 * 12_544
    active = attention + 3 * 2048 * 8192 + 4 * sparse + head
    assert by_kind.active_matmul_params(LAGUNA) == active == 275_841_024
    scores = (
        2 * 4 * 48 * 128 * 2048
        + 3 * 4 * 64 * 128 * by_kind.keys_per_query(SEQ, 512)
    )
    assert by_kind.attention_score_flops_per_token(LAGUNA, SEQ) == scores
    forward = by_kind.forward_flops_per_token(LAGUNA, SEQ)
    assert forward == 2 * active + scores == 699_537_408
    assert by_kind.train_flops_per_token(LAGUNA, SEQ) == 3 * forward
    # attention is 70 % of it: projections and gate 345 M, scores 148 M
    assert round(2 * attention / 1e6) == 345 and round(scores / 1e6) == 148
    assert round(100 * (2 * attention + scores) / forward) == 70
    # what costs.py counts for this file: one head count, the causal half
    one_kind = costs.train_flops_per_token(LAGUNA, SEQ) / 3
    assert round(one_kind / 1e6, 1) == 751.8
    assert round(100 * (one_kind / forward - 1), 1) == 7.5
    # 691.6 M parameters: what is multiplied, the other 31 held experts of
    # four layers and the embedding, less the router counted at its width
    held = 4 * 31 * expert
    assert round((active + held + head) / 1e6, 1) == 691.6


def test_the_flash_kernels_by_kind_by_hand():
    window = by_kind.flash_train(LAGUNA, SEQUENCES, SEQ, by_kind.SLIDING)
    full = by_kind.flash_train(LAGUNA, SEQUENCES, SEQ, by_kind.FULL)
    keys = by_kind.keys_per_query(SEQ, 512)
    assert window["flops"] == 3 * 3.5 * 2.0 * TOKENS * 64 * keys * 256
    assert full["flops"] == 2 * 3.5 * 2.0 * TOKENS * 48 * 2048 * 256
    # q, k, v, o whole whatever the window: 3 passes over q, k, v; 3 of o
    a_layer = lambda h: 3 * TOKENS * (h + 2 * 8) * 128 * 2 \
        + 3 * TOKENS * h * 128 * 2
    assert window["bytes"] == 3 * a_layer(64)
    assert full["bytes"] == 2 * a_layer(48)
    one = costs.flash_train(LAGUNA, SEQUENCES, SEQ)
    assert round(5 * one["flops"] / (window["flops"] + full["flops"]), 2) \
        == 1.70
    # both kinds are compute-bound on the v5e
    assert costs.roofline_seconds(window, PEAK)[1] == "compute"
    assert costs.roofline_seconds(full, PEAK)[1] == "compute"


@pytest.mark.parametrize("name,sequences", [
    ("qwen3-30b-a3b-l1", 4), ("xing4.0-29b-a4b-share8", 2),
    ("deepseek-v2-lite-l2", 4), ("qwen3-30b-a3b-ep4", 4),
])
def test_a_stack_of_one_kind_equals_costs_py_to_the_digit(name, sequences):
    cfg = config(name)
    assert by_kind.active_matmul_params(cfg) == costs.active_matmul_params(cfg)
    assert by_kind.attention_score_flops_per_token(cfg, SEQ) \
        == costs.attention_score_flops_per_token(cfg, SEQ)
    assert by_kind.train_flops_per_token(cfg, SEQ) \
        == costs.train_flops_per_token(cfg, SEQ)
    layers_trained = costs.n_trained_attention_layers(cfg)
    assert len(by_kind.trained_layers(cfg)) == layers_trained
    one = costs.flash_train(cfg, sequences, SEQ)
    assert by_kind.flash_train(cfg, sequences, SEQ, by_kind.FULL) == {
        k: v * layers_trained for k, v in one.items()}
    assert by_kind.flash_train(cfg, sequences, SEQ, by_kind.SLIDING) == {
        "flops": 0.0, "bytes": 0.0}


def test_the_share_cut_reads_these_costs():
    """What ``costs.py`` says of this file where the cell is listed on its
    metrics: the held experts' matmuls, every digit."""
    assert costs.n_dense_layers(LAGUNA) == 1
    assert costs.n_trained_sparse_layers(LAGUNA) == 4
    assert costs.n_routed_experts(LAGUNA) == 32
    assert costs.published_experts(LAGUNA) == 256
    assert costs.routed_per_token(LAGUNA) == 1.0
    assert costs.expert_mm_train(LAGUNA, TOKENS) == {
        "flops": 3 * 2.0 * TOKENS * 3 * 2048 * 512,
        "bytes": 3.0 * 32 * 3 * 2048 * 512 * 2
        + 3.0 * TOKENS * (2 * 2048 + 4 * 512) * 2,
    }


# -- the readers ---------------------------------------------------------------


def span(name, t0, dur_s, step, meta=None):
    return types.SimpleNamespace(
        name=name, t0=t0, dur_s=dur_s, step=step, meta=meta)


def run_of(cfg=LAGUNA, steps=80, **observed):
    cell = types.SimpleNamespace(config=cfg)
    o = types.SimpleNamespace(
        opened_at=10.0, closed_at=50.0, tokens_per_step=TOKENS, chips=1,
        seq_len=SEQ, steps_in_window=steps, window_s=40.0, **observed,
    )
    return readers.Run(cell=cell, observed=o, setup_s=0.0, inventory=(),
                       device_kind="TPU v5 lite")


def test_mfu_by_kind_is_the_rate_times_the_per_kind_count():
    run = run_of()
    rate = 80 * TOKENS / 40.0
    assert readers.read(run, "train.mfu_by_kind_pct") == pytest.approx(
        100 * 3 * 699_537_408 * rate / 197e12)
    assert 0 < readers.read(run, "train.mfu_by_kind_pct") < 100
    # a file that states no kinds has train.mfu_pct for this
    assert readers.read(
        run_of(config("qwen3-30b-a3b-l1")), "train.mfu_by_kind_pct") is None


COUNTS = {
    "flash/window/fwd/blocks_visited": 8192.0,
    "flash/window/fwd/blocks_computed": 2816.0,
    "flash/window/bwd/blocks_visited": 16384.0,
    "flash/window/bwd/blocks_computed": 5632.0,
    "flash/full/fwd/blocks_visited": 6144.0,
    "flash/full/fwd/blocks_computed": 3840.0,
}


def test_blocks_computed_is_the_window_kinds_counts(monkeypatch):
    spans = [
        span("train/step", 5.0, 0.4, 2, {
            "flash/window/fwd/blocks_visited": 1.0}),  # warm-up
        span("train/step", 11.0, 0.4, 10, dict(COUNTS, **{"moe/rows_held": 1.0})),
        span("train/step", 12.0, 0.4, 11),  # a step that fetched nothing
        span("train/step", 15.0, 0.4, 20, COUNTS),
    ]
    monkeypatch.setattr(layers, "program_spans", lambda: list(spans))
    # 4 x 64 grids of 4 x 8 pairs, 11 in reach of the window: 34.4 %
    assert readers.read(run_of(), "kernel.flash_window_blocks_computed_pct") \
        == pytest.approx(100 * 11 / 32)
    # the parent's spans carry no such counts; nor does a program whose
    # attention is of one kind with no window
    monkeypatch.setattr(layers, "program_spans", lambda: [
        span("train/step", 11.0, 0.4, 10, {"moe/rows_held": 1.0}),
        span("train/step", 12.0, 0.4, 11, {
            k: v for k, v in COUNTS.items() if "/full/" in k}),
    ])
    assert readers.read(
        run_of(), "kernel.flash_window_blocks_computed_pct") is None


JIT = "jit(step)/jit(main)/"
SCOPES = {
    "custom-call.1": JIT + "jvp(LagunaCausalLM)/model/layers_0/self_attn/"
                           "self_attn._sdpa_padded/pallas_call",
    "custom-call.2": JIT + "jvp(LagunaCausalLM)/model/layers_1/attn_window/"
                           "self_attn/self_attn._sdpa_padded/pallas_call",
    "custom-call.3": JIT + "transpose(jvp(LagunaCausalLM))/model/layers_1/"
                           "attn_window/self_attn/pallas_call",
    "custom-call.4": JIT + "transpose(jvp(LagunaCausalLM))/model/layers_4/"
                           "self_attn/self_attn._sdpa_padded/pallas_call",
    "custom-call.5": JIT + "jvp(LagunaCausalLM)/model/layers_1/attn_window/"
                           "self_attn/rope/mul",
    "custom-call.6": JIT + "jvp(LagunaCausalLM)/model/layers_1/mlp/moe/"
                           "experts/down",
}
SECONDS = {"custom-call.1": 10e-3, "custom-call.2": 12e-3,
           "custom-call.3": 30e-3, "custom-call.4": 25e-3,
           "custom-call.5": 2e-3, "custom-call.6": 40e-3}


def hlo(scope_of: dict) -> str:
    lines = "\n".join(
        f'  %{name} = bf16[4,64,4096,128]{{3,2,1,0}} custom-call(%p0), '
        f'custom_call_target="tpu_custom_call", '
        f'metadata={{op_name="{scope}"}}'
        for name, scope in scope_of.items()
    )
    return (
        "HloModule jit_step\n\n"
        "ENTRY %main (p0: bf16[8]) -> bf16[8] {\n"
        "  %p0 = bf16[8]{0} parameter(0)\n" + lines + "\n}\n"
    )


def traced_run(scope_of=SCOPES, cfg=LAGUNA):
    """A hand-made trace of one execution of the step program: one event
    an instruction, end to end, under the module ``jit_step``."""
    result = "bf16[4,64,4096,128]{3,2,1,0}"
    ops, t = [], 0.0
    for name, dur in SECONDS.items():
        ops.append((f"%{name} = {result} custom-call(%p0)", t, dur))
        t += dur
    run = run_of(cfg)
    run.trace = {
        "devices": {"/device:TPU:0": {
            "ops": ops, "modules": [("jit_step(123)", 0.0, t)],
        }},
        "host": [],
    }
    run.programs = (layers.compiled_program(hlo(scope_of)),)
    return run


def test_each_kinds_roofline_takes_its_own_calls():
    run = traced_run()
    window = by_kind.flash_train(LAGUNA, SEQUENCES, SEQ, by_kind.SLIDING)
    full = by_kind.flash_train(LAGUNA, SEQUENCES, SEQ, by_kind.FULL)
    got = readers.read(run, "kernel.flash_window_train_roofline")
    assert got == pytest.approx(
        100 * costs.roofline_seconds(window, PEAK)[0] / 42e-3)
    assert run.notes["kernel.flash_window_train_roofline.bound"] == "compute"
    got = readers.read(run, "kernel.flash_full_train_roofline")
    assert got == pytest.approx(
        100 * costs.roofline_seconds(full, PEAK)[0] / 35e-3)
    assert run.notes["kernel.flash_full_train_roofline.bound"] == "compute"
    # a program whose layers are all of the plain kind: the window's reads
    # nothing, the full kind's every call
    plain = {k: v.replace("attn_window/", "") for k, v in SCOPES.items()}
    run = traced_run(plain)
    assert readers.read(run, "kernel.flash_window_train_roofline") is None
    assert readers.read(run, "kernel.flash_full_train_roofline") \
        == pytest.approx(
            100 * costs.roofline_seconds(full, PEAK)[0] / 77e-3)
    # no trace, or a file that states no kinds: nothing
    for name in OWN[1:3]:
        assert readers.read(run_of(), name) is None
        assert readers.read(
            traced_run(cfg=config("qwen3-30b-a3b-l1")), name) is None


def test_the_window_layers_share_is_their_scopes_self_time():
    run = traced_run()
    got = readers.read(run, "model.train_window_attention_device_pct")
    assert got == pytest.approx(100 * 44e-3 / 119e-3)
    plain = {k: v.replace("attn_window/", "") for k, v in SCOPES.items()}
    assert readers.read(
        traced_run(plain), "model.train_window_attention_device_pct") is None
    assert readers.read(
        run_of(), "model.train_window_attention_device_pct") is None
