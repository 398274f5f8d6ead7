"""The ZAYA1-8B serving cell end to end at tiny widths on the CPU rig, a
new process per run as the driver starts it: the contract's last line,
``correct`` true against the family's reference (which reads every size
from the tree there), counters only; the three tails' gauge and the
skip's count from one chunk; the manifest's entries for the cell, found
by name."""

import pytest

from tests.conftest import load_repo_module

# the helpers of the first tiny-run tests: one run per module and case
_tiny = load_repo_module("bench_run_tiny", "tests/benchmarks/test_run_tiny.py")
COUNTERS, tiny_line, in_order = _tiny.COUNTERS, _tiny.tiny_line, _tiny.in_order
CELL = "zaya1-8b-decode.serve-reason-closed"
SOLAR = "solar-open2-250b-share8-decode.serve-reason-closed"
MIMO = "mimo-v2-flash-share16-decode.serve-reason-closed"
OWN = ["model.decode_cca_mix_device_pct", "model.decode_router_device_pct",
       "moe.decode_skip_rows_pct"]


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
    # zaya_tiny: 4 layers x 4 slots x (two tails of 6 heads x 16 and a late
    # value head of 16) bf16 numbers
    state = metrics["serve.recurrent_state_gb"]
    assert state["unit"] == "GB"
    assert state["value"] == pytest.approx(4 * 4 * (96 + 96 + 16) * 2 / 1e9)
    # 4 experts and a skip: 20 % at an even router; what is not skipped is
    # held, every expert being here
    skipped = metrics["moe.decode_skip_rows_pct"]
    held = metrics["moe.decode_held_rows_pct"]
    assert skipped["unit"] == "%" and 2.0 <= skipped["value"] <= 45.0
    assert held["value"] == pytest.approx(100.0 - skipped["value"])
    # the tiny table: prompts 3 and 6, outputs 10 and 20
    context = metrics["serve.mean_context_tokens"]
    assert 6.5 <= context["value"] <= 13.0
    # shares of device time and of a roofline come from a device trace
    assert not {OWN[0], OWN[1], "kernel.gqa_decode_roofline"} & set(metrics)


def test_the_manifest_gives_the_cell_its_metrics():
    from benchmarks.harness import manifest

    cell = manifest.cell(CELL)
    solar, mimo = manifest.cell(SOLAR), manifest.cell(MIMO)
    names = [m["name"] for m in cell.per_layer]
    # what every serving cell reports, the experts' pair of the MoE
    # serving cells, the state's gauge of the cells with per-row state,
    # the held range's count
    assert set(_tiny.EVERY_SERVING_CELL) <= set(names)
    assert set(_tiny.EXPERT_SERVING_CELLS) <= set(names)
    assert in_order(
        ["model.decode_experts_device_pct", "serve.mean_context_tokens",
         "serve.recurrent_state_gb", "kernel.gqa_decode_roofline",
         "moe.decode_held_rows_pct",
         "serve.reset_rows_ms_per_admitting_chunk", *OWN],
        names)
    # its own three are not Solar's nor MiMo's
    assert not set(OWN) & {
        m["name"] for m in solar.per_layer + mimo.per_layer}
    # the paged decode kernel's roofline is MiMo's and this cell's
    assert "kernel.gqa_decode_roofline" in {m["name"] for m in mimo.per_layer}
    assert "kernel.gqa_decode_roofline" not in {
        m["name"] for m in solar.per_layer}
    # another recurrence's scopes and counts, the window layers' and the
    # latent pool's: nothing to read here
    absent = {"model.decode_ssm_device_pct", "kernel.ssm_decode_roofline",
              "kernel.ssm2_decode_roofline", "kernel.kda_decode_roofline",
              "model.decode_kda_device_pct", "serve.window_cache_gb",
              "kernel.mla_decode_roofline",
              "model.decode_window_attention_device_pct",
              "serve.latent_pool_used_pct"}
    assert not absent & set(names)
    assert [m["name"] for m in cell.end_to_end] == [
        "serve_tokens_per_s", "serve_ttft_p95_ms", "serve_tpot_p95_ms",
        "setup_s",
    ]
    assert cell.chips == 1
    # depth alone is cut: no share, every expert, the whole table
    assert cell.config["reduced"] == ["num_hidden_layers"]
    assert "share" not in cell.config
    assert (cell.config["num_experts"], cell.config["vocab_size"]) == (
        16, 262_272)
    assert cell.config["serving"] == {
        "slots": 256, "page_size": 64, "decode_max_length": 1152,
    }
    assert len(cell.config["layer_types"]) == 40  # kept as published
    assert cell.traffic_name == "serve-reason-closed"
    # the same table of requests as the other reasoning cells
    assert cell.traffic == solar.traffic == mimo.traffic
