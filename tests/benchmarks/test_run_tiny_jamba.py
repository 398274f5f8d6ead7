"""The Jamba2-3B serving cell end to end at tiny widths on the CPU rig,
a new process per run as the driver starts it: the contract's last line,
``correct`` true against the family's reference (which reads the layer
kinds from the tree there), counters only, and the cell's own counter
metric read from the program."""

import pytest

from tests.conftest import load_repo_module

# the helpers of the first tiny-run tests: one run per module and case
_tiny = load_repo_module("bench_run_tiny", "tests/benchmarks/test_run_tiny.py")
COUNTERS, tiny_line, in_order = _tiny.COUNTERS, _tiny.tiny_line, _tiny.in_order
ROOT = _tiny.ROOT
CELL = "jamba2-3b-decode.serve-reason-closed"

SHARED = [
    "entry.compile_s", "entry.serve_compiles_in_window",
    "serve.slot_occupancy_pct", "serve.prompt_step_share_pct",
    "serve.ttft_p50_ms", "serve.tpot_p50_ms", "serve.host_gap_ms_per_chunk",
    "device.serve_idle_pct", "serve.phase_host_ms_per_chunk",
    "serve.longest_chunk_ms", "serve.gc_pause_ms",
    "serve.prompt_slot_steps_pct", "entry.lower_s",
    "model.decode_attention_device_pct", "serve.mean_context_tokens",
]
OWN = [
    "model.decode_ssm_device_pct", "kernel.ssm_decode_roofline",
    "serve.recurrent_state_gb",
]


@pytest.mark.parametrize("trace", [0, 2])
def test_tiny_run_prints_the_contracts_last_line(trace):
    line = tiny_line(CELL, trace, 1)
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    assert line["device"]["memory_peak_bytes"] > 0
    # a CPU run gives counts only: no time, rate, share of a peak or trace
    assert set(line["metrics"]) <= COUNTERS
    assert "busy_s" not in line["device"] and "breakdown" not in line


def test_the_cells_own_counter_is_read_from_the_program():
    line = tiny_line(CELL, 2, 1)
    metrics = line["metrics"]
    assert metrics["entry.serve_compiles_in_window"]["value"] == 0.0
    # jamba_tiny: 1 mixer x 4 slots x 128 channels x (16 state numbers
    # of 4 B + 3 tail rows of 2 B)
    state = metrics["serve.recurrent_state_gb"]
    assert state["unit"] == "GB"
    assert state["value"] == pytest.approx(4 * 128 * (16 * 4 + 3 * 2) / 1e9)
    # the tiny table: prompts 3 and 6, outputs 10 and 20
    context = metrics["serve.mean_context_tokens"]
    assert 6.5 <= context["value"] <= 13.0
    # shares of device time and of a roofline come from a device trace
    assert "kernel.ssm_decode_roofline" not in metrics
    assert "model.decode_ssm_device_pct" not in metrics


def check_the_manifest_gives_the_cell_its_metrics(root=ROOT):
    """A later PR that drops the cell from a list fails here and not in
    the driver's check (a listed metric missing from the last line is
    ``output_malformed``, one never listed is never read)."""
    from benchmarks.harness import manifest

    cell = manifest.cell(CELL, root=root)
    glm = manifest.cell("glm-4.7-flash-decode.serve-reason-closed", root=root)
    names = [m["name"] for m in cell.per_layer]
    assert in_order(SHARED + OWN, names)
    assert set(_tiny.EVERY_SERVING_CELL) <= set(names)
    # what the other serving cells report and this one has nothing to read for
    absent = {"kernel.expert_mm_decode_roofline", "kernel.mla_decode_roofline",
              "model.decode_experts_device_pct", "serve.latent_pool_used_pct"}
    assert absent <= {m["name"] for m in glm.per_layer}
    assert not absent & set(names)
    assert [m["name"] for m in cell.end_to_end] == [
        "serve_tokens_per_s", "serve_ttft_p95_ms", "serve_tpot_p95_ms",
        "setup_s",
    ]
    # its own three are read from recurrent state: the cells of PR 43 that
    # keep none are not listed (a later cell that keeps some may be)
    stateless = {glm.name, "qwen3-30b-a3b-decode.serve-rollout-closed",
                 "mimo-v2-flash-share16-decode.serve-reason-closed"}
    for metric in cell.per_layer:
        if metric["name"] in OWN:
            assert not stateless & set(metric["workloads"])
    assert cell.chips == 1 and cell.config["reduced"] == []
    assert cell.config["serving"] == {
        "slots": 256, "page_size": 64, "decode_max_length": 1152,
    }
    assert cell.traffic_name == "serve-reason-closed"
    assert cell.traffic["kind"] == "closed_loop"
    # the same table of requests as the latent-pool cell
    assert cell.traffic == glm.traffic


def test_the_manifest_gives_the_cell_its_metrics():
    check_the_manifest_gives_the_cell_its_metrics()
