"""The Solar-Open2-250B serving cell end to end at tiny widths on the CPU
rig, a new process per run as the driver starts it: the contract's last
line, ``correct`` true against the family's reference (which reads the
layer kinds and the share from the tree there), counters only; the
recurrent state's gauge and the held range's counts from one chunk; the
manifest's entries for the cell, found by name."""

import pytest

from tests.conftest import load_repo_module

# the helpers of the first tiny-run tests: one run per module and case
_tiny = load_repo_module("bench_run_tiny", "tests/benchmarks/test_run_tiny.py")
COUNTERS, tiny_line, in_order = _tiny.COUNTERS, _tiny.tiny_line, _tiny.in_order
CELL = "solar-open2-250b-share8-decode.serve-reason-closed"
GRANITE = "granite-4.0-h-small-share4-decode.serve-reason-closed"
JAMBA = "jamba2-3b-decode.serve-reason-closed"
# the KDA mixers' roofline and share (listed since PR 53) and the
# admission's reset, which the cells that keep recurrent state share
KDA = ["kernel.kda_decode_roofline", "model.decode_kda_device_pct"]
RESET = "serve.reset_rows_ms_per_admitting_chunk"


def test_tiny_run_prints_the_contracts_last_line_and_its_counters():
    # one run, as the driver's traced runs are made (``--trace 2`` measures
    # as ``--trace 0`` does, then traces): a tiny run is 70 s of CPU
    line = tiny_line(CELL, 2, 1)
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    assert line["device"]["memory_peak_bytes"] > 0
    # a CPU run gives counts only: no time, rate, share of a peak or trace
    metrics = line["metrics"]
    assert set(metrics) <= COUNTERS
    assert "busy_s" not in line["device"] and "breakdown" not in line
    assert metrics["entry.serve_compiles_in_window"]["value"] == 0.0
    # solar_tiny: 3 mixers x 4 slots x (4 heads x 16 x 16 state numbers of
    # 4 B + 3 tail rows of 3 x 64 channels of 2 B)
    state = metrics["serve.recurrent_state_gb"]
    assert state["unit"] == "GB"
    assert state["value"] == pytest.approx(
        3 * 4 * (4 * 16 * 16 * 4 + 3 * 192 * 2) / 1e9)
    # 4 of 16 routed experts held: 25 % at an even router
    held = metrics["moe.decode_held_rows_pct"]
    assert held["unit"] == "%" and 10.0 <= held["value"] <= 45.0
    # the tiny table: prompts 3 and 6, outputs 10 and 20
    context = metrics["serve.mean_context_tokens"]
    assert 6.5 <= context["value"] <= 13.0
    # shares of device time and of a roofline come from a device trace
    assert not {*KDA, RESET} & set(metrics)


def test_the_manifest_gives_the_cell_its_metrics():
    from benchmarks.harness import manifest

    cell = manifest.cell(CELL)
    granite, jamba = manifest.cell(GRANITE), manifest.cell(JAMBA)
    names = [m["name"] for m in cell.per_layer]
    # what every serving cell reports, the experts' share of the MoE
    # serving cells, the state's gauge of the Jamba and Granite cells, the
    # held range's count of the share cells
    assert set(_tiny.EVERY_SERVING_CELL) <= set(names)
    # the expert products' roofline takes this cell, as it does not take
    # Granite's: 1.26 GB of held experts a layer do not fit fast memory,
    # no operand is copied there outside the products' own time, and the
    # share read 79.06 to 79.07 in three traced runs (PERF.md section 6)
    assert set(_tiny.EXPERT_SERVING_CELLS) <= set(names)
    assert "kernel.expert_mm_decode_roofline" not in {
        m["name"] for m in granite.per_layer}
    assert in_order(
        ["model.decode_experts_device_pct", "serve.mean_context_tokens",
         "serve.recurrent_state_gb", "moe.decode_held_rows_pct"],
        names)
    assert in_order(["moe.decode_held_rows_pct", *KDA, RESET], names)
    assert not set(KDA) & {
        m["name"] for m in granite.per_layer + jamba.per_layer}
    assert RESET in {m["name"] for m in granite.per_layer} \
        & {m["name"] for m in jamba.per_layer}
    # Mamba's scope and counts, the window layers' and the latent pool's:
    # nothing to read here
    absent = {"model.decode_ssm_device_pct", "kernel.ssm_decode_roofline",
              "kernel.ssm2_decode_roofline", "kernel.gqa_decode_roofline",
              "serve.window_cache_gb", "kernel.mla_decode_roofline",
              "model.decode_window_attention_device_pct",
              "serve.latent_pool_used_pct"}
    assert not absent & set(names)
    assert [m["name"] for m in cell.end_to_end] == [
        "serve_tokens_per_s", "serve_ttft_p95_ms", "serve_tpot_p95_ms",
        "setup_s",
    ]
    assert cell.chips == 1
    assert cell.config["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert cell.config["share"] == {
        "published": {"n_routed_experts": 320, "vocab_size": 196_608}}
    assert cell.config["serving"] == {
        "slots": 256, "page_size": 64, "decode_max_length": 1152,
    }
    assert len(cell.config["gqa_layers"]) == 12
    assert cell.config["linear_attn_config"]["num_kv_heads"] is None
    assert cell.traffic_name == "serve-reason-closed"
    # the same table of requests as the other reasoning cells
    assert cell.traffic == granite.traffic == jamba.traffic
