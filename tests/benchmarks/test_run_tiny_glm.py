"""The GLM-4.7-Flash serving cell end to end at tiny widths on the CPU
rig, a new process per run as the driver starts it: the contract's last
line, ``correct`` true against the family's reference, counters only,
and the three metrics this cell brings where the program has the
counters they read."""

import pytest

from tests.conftest import load_repo_module

# the helpers of the first tiny-run tests: one run per module and case
_tiny = load_repo_module("bench_run_tiny", "tests/benchmarks/test_run_tiny.py")
COUNTERS, tiny_line, in_order = _tiny.COUNTERS, _tiny.tiny_line, _tiny.in_order
ROOT, SHARED = _tiny.ROOT, _tiny.EVERY_SERVING_CELL + _tiny.EXPERT_SERVING_CELLS
OWN = ["kernel.mla_decode_roofline", "serve.mean_context_tokens",
       "serve.latent_pool_used_pct"]
CELL = "glm-4.7-flash-decode.serve-reason-closed"


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


def test_the_cells_own_counters_are_read_from_the_program():
    line = tiny_line(CELL, 2, 1)
    metrics = line["metrics"]
    assert metrics["entry.serve_compiles_in_window"]["value"] == 0.0
    # the tiny table: prompts 3 and 6, outputs 10 and 20, so a request
    # holds its slot 12 to 25 steps and its mean context is 6.5 to 13
    context = metrics["serve.mean_context_tokens"]
    assert context["unit"] == "tokens" and 6.5 <= context["value"] <= 13.0
    # 4 slots x 4 pages of 8 positions; a request holds 2 to 4 of them
    used = metrics["serve.latent_pool_used_pct"]
    assert used["unit"] == "%" and 50.0 <= used["value"] <= 100.0
    # a share of a roofline comes from a device trace: never on the CPU
    assert "kernel.mla_decode_roofline" not in metrics


def check_the_manifest_gives_the_cell_its_metrics(root=ROOT):
    from benchmarks.harness import manifest

    cell = manifest.cell(CELL, root=root)
    rollout = manifest.cell(
        "qwen3-30b-a3b-decode.serve-rollout-closed", root=root)
    names = [m["name"] for m in cell.per_layer]
    # every serving metric the rollout cell reports, and three of its own
    # in the manifest's order
    assert set(SHARED) <= set(names)
    assert set(SHARED) <= {m["name"] for m in rollout.per_layer}
    assert in_order(OWN, names)
    assert not {OWN[0], OWN[2]} & {m["name"] for m in rollout.per_layer}
    assert [m["name"] for m in cell.end_to_end] == \
        [m["name"] for m in rollout.end_to_end]
    assert cell.config["serving"] == {
        "slots": 64, "page_size": 64, "decode_max_length": 1152,
    }
    assert cell.traffic["kind"] == "closed_loop"


def test_the_manifest_gives_the_cell_its_metrics():
    check_the_manifest_gives_the_cell_its_metrics()
