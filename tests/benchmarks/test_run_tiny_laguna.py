"""The Laguna-XS.2 training cell end to end at tiny widths on the CPU
rig, a new process as the driver starts it: the contract's last line,
``correct`` true against the family's reference (which reads the layer
kinds from the tree there) on a sample four tiny windows long, counters
only; the manifest's entry for the cell, its traffic file, and the cell's
own five metrics, listed for it alone."""

import pytest

from tests.conftest import load_repo_module

# the helpers of the first tiny-run tests: one run per module and case
_tiny = load_repo_module("bench_run_tiny", "tests/benchmarks/test_run_tiny.py")
COUNTERS, tiny_line, in_order = _tiny.COUNTERS, _tiny.tiny_line, _tiny.in_order
ROOT = _tiny.ROOT
CELL = "laguna-xs.2-share8.train-16k-sample4k"
XING = "xing4.0-29b-a4b-share8.train-8k"
L1 = "qwen3-30b-a3b-l1.train-16k"

# what the cell reports of the training metrics, in the manifest's order:
# all but the two that harness/costs.py counts at one head count and the
# causal half of the sequence for every layer
SHARED = [
    "entry.compile_s", "entry.train_compiles_in_window",
    "train.host_unthrottled_step_pct", "step.train_step_device_ms",
    "step.hbm_claim_gb", "kernel.expert_mm_train_roofline",
    "device.train_idle_pct", "entry.lower_s",
    "model.train_experts_device_pct", "model.train_attention_device_pct",
    "model.train_head_loss_device_pct", "model.train_optimizer_device_pct",
    "moe.held_rows_pct",
]
NOT_THIS_CELLS = ["train.mfu_pct", "kernel.flash_train_roofline"]
OWN = [
    "train.mfu_by_kind_pct", "kernel.flash_window_train_roofline",
    "kernel.flash_full_train_roofline",
    "model.train_window_attention_device_pct",
    "kernel.flash_window_blocks_computed_pct",
]


def test_tiny_run_prints_the_contracts_last_line():
    line = tiny_line(CELL, 2, 1)
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    assert line["device"]["memory_peak_bytes"] > 0
    # a CPU run gives counts only: no time, rate, share of a peak or trace
    assert set(line["metrics"]) <= COUNTERS
    assert "busy_s" not in line["device"] and "breakdown" not in line


def test_the_cells_counters_are_read_from_the_program():
    metrics = tiny_line(CELL, 2, 1)["metrics"]
    assert metrics["entry.train_compiles_in_window"]["value"] == 0.0
    assert metrics["step.hbm_claim_gb"]["value"] > 0
    # laguna_tiny holds 4 of 32 routed experts: 12.5 % at an even router
    held = metrics["moe.held_rows_pct"]
    assert held["unit"] == "%" and 8.0 <= held["value"] <= 18.0
    # the CPU rig attends through the eager backend: no flash grid is
    # traced, so the wrapper's counts are not there to read
    # (test_attention_kinds_train_cost.py has the reader on hand-made
    # spans, tests/models/test_laguna.py the counts of a traced call)
    assert "kernel.flash_window_blocks_computed_pct" not in metrics
    # a rate, shares of device time and of a roofline come from a chip
    assert not set(OWN) & set(metrics)


def check_the_manifest_gives_the_cell_its_metrics(root=ROOT):
    """A later PR that drops the cell from a list fails here and not in
    the driver's check (a listed metric missing from the last line is
    ``output_malformed``, one never listed is never read)."""
    from benchmarks.harness import manifest

    cell = manifest.cell(CELL, root=root)
    xing = manifest.cell(XING, root=root)
    names = [m["name"] for m in cell.per_layer]
    assert in_order(SHARED, names) and in_order(OWN, names)
    assert not set(NOT_THIS_CELLS) & set(names)
    # what the other training cells report and this one has nothing for
    assert not {
        "shard.collective_exposed_pct", "kernel.mhc_train_roofline",
        "model.train_residual_mix_device_pct", "model.train_mtp_device_pct",
        "moe.ep_buffer_fill_pct", "moe.ep_fallback_pct",
    } & set(names)
    assert not any(n.startswith(("serve.", "model.decode")) for n in names)
    assert set(SHARED) <= {m["name"] for m in xing.per_layer}
    assert [m["name"] for m in cell.end_to_end] == [
        "train_tokens_per_s_per_chip", "setup_s"]
    assert cell.chips == 1
    assert cell.config["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert cell.config["share"] == {
        "published": {"num_experts": 256, "vocab_size": 100_352}}
    assert (cell.config["num_hidden_layers"], cell.config["num_experts"],
            cell.config["vocab_size"]) == (5, 32, 12_544)
    assert len(cell.config["layer_types"]) == 40
    assert len(cell.config["num_attention_heads_per_layer"]) == 40
    for key in ("router_score", "shared_expert_gate", "qk_norm", "gating",
                "first_k_dense_replace", "n_shared_experts"):
        assert key in cell.config["assumed"], key
    assert "rope_theta" not in cell.config
    # the traffic: train-16k to the letter in the timed window, with the
    # comparison's sample one timed sequence long (eight windows deep;
    # tiny: 64 tokens against the tiny preset's window of 16)
    assert cell.traffic_name == "train-16k-sample4k"
    l1 = manifest.cell(L1, root=root)
    differ = {"why", "trainer_why", "sample_tokens", "tiny"}
    assert {k: v for k, v in cell.traffic.items() if k not in differ} \
        == {k: v for k, v in l1.traffic.items() if k not in differ}
    assert cell.traffic["sample_tokens"] == cell.traffic["seq_len"] == 4096
    assert l1.traffic["sample_tokens"] == 512
    assert cell.traffic["tiny"] == dict(
        l1.traffic["tiny"], sample_tokens=64)


def test_the_manifest_gives_the_cell_its_metrics():
    check_the_manifest_gives_the_cell_its_metrics()


OTHERS_AT_PR_44 = [
    L1, "qwen3-30b-a3b-decode.serve-rollout-closed",
    "deepseek-v2-lite-l2.train-16k", "qwen3-30b-a3b-ep4.train-16k",
    "glm-4.7-flash-decode.serve-reason-closed",
    "jamba2-3b-decode.serve-reason-closed", XING,
    "mimo-v2-flash-share16-decode.serve-reason-closed",
]
OWN_FILES = [
    ("train.mfu_by_kind_pct", "host_clock", "training loop", "higher"),
    ("kernel.flash_window_train_roofline", "device_trace", "kernels",
     "higher"),
    ("kernel.flash_full_train_roofline", "device_trace", "kernels", "higher"),
    ("model.train_window_attention_device_pct", "device_trace", "model",
     "lower"),
    ("kernel.flash_window_blocks_computed_pct", "program_counter", "kernels",
     "higher"),
]


def check_an_own_metrics_file_is_listed_for_its_cell(
        name, source, layer, better, root=ROOT):
    """Entries alone: a file holds what its entry repeats, and a reader
    (``test_attention_kinds_train_cost.py`` has each over a hand-made
    run)."""
    from benchmarks.harness import manifest

    assert name in OWN
    by_name = {m["name"]: m for m in manifest.manifest(root)["per_layer"]}
    entry = by_name[name]
    # for this cell and for none of the other cells of PR 44: none of them
    # trains through a stack that mixes attention kinds (a later cell that
    # does may join)
    assert CELL in entry["workloads"]
    assert not set(OTHERS_AT_PR_44) & set(entry["workloads"])
    own = manifest.metric_file(name, root=root)
    assert own["name"] == name and own["reader"] == {"file": True}
    assert (root / "benchmarks/metrics" / f"{name}.py").is_file()
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == own[key], key
    assert (own["unit"], own["source"], own["layer"], own["moves"],
            own["better"]) == (
        "%", source, layer, "train_tokens_per_s_per_chip", better)
    assert not {"kinds", "min_chips", "workloads"} & set(own)


@pytest.mark.parametrize("name,source,layer,better", OWN_FILES)
def test_an_own_metrics_file_is_listed_for_its_cell(
        name, source, layer, better):
    check_an_own_metrics_file_is_listed_for_its_cell(
        name, source, layer, better)
