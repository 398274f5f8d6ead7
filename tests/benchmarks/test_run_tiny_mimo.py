"""The MiMo-V2-Flash serving cell end to end at tiny widths on the CPU
rig, a new process per run as the driver starts it: the contract's last
line, ``correct`` true against the family's reference (which reads the
layer kinds from the tree there), counters only; the manifest's entry
for the cell, and the cell's own four metrics, listed for it alone."""

import pytest

from tests.conftest import load_repo_module

# the helpers of the first tiny-run tests: one run per module and case
_tiny = load_repo_module("bench_run_tiny", "tests/benchmarks/test_run_tiny.py")
COUNTERS, tiny_line, in_order = _tiny.COUNTERS, _tiny.tiny_line, _tiny.in_order
ROOT = _tiny.ROOT
CELL = "mimo-v2-flash-share16-decode.serve-reason-closed"
JAMBA = "jamba2-3b-decode.serve-reason-closed"

OWN = [
    "serve.window_cache_gb", "model.decode_window_attention_device_pct",
    "kernel.gqa_decode_roofline", "moe.decode_held_rows_pct",
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


def test_the_cells_counters_are_read_from_the_program():
    line = tiny_line(CELL, 2, 1)
    metrics = line["metrics"]
    assert metrics["entry.serve_compiles_in_window"]["value"] == 0.0
    # the tiny table: prompts 3 and 6, outputs 10 and 20
    context = metrics["serve.mean_context_tokens"]
    assert 6.5 <= context["value"] <= 13.0
    # of the cell's own four the two counters are printed. mimo_v2_flash_tiny:
    # 2 window layers x 4 slots x a ring of 12 positions x 4 key/value
    # heads x (24 + 16) bf16 numbers
    rings = metrics["serve.window_cache_gb"]
    assert rings["unit"] == "GB"
    assert rings["value"] == pytest.approx(2 * 4 * 12 * 4 * (24 + 16) * 2 / 1e9)
    # 4 of 64 routed experts held: 6.25 % at an even router
    held = metrics["moe.decode_held_rows_pct"]
    assert held["unit"] == "%" and 3.0 <= held["value"] <= 12.5
    # shares of device time and of a roofline come from a device trace
    assert "kernel.gqa_decode_roofline" not in metrics
    assert "model.decode_window_attention_device_pct" not in metrics


def check_the_manifest_gives_the_cell_its_metrics(root=ROOT):
    """A later PR that drops the cell from a list fails here and not in
    the driver's check (a listed metric missing from the last line is
    ``output_malformed``, one never listed is never read)."""
    from benchmarks.harness import manifest

    cell = manifest.cell(CELL, root=root)
    jamba = manifest.cell(JAMBA, root=root)
    glm = manifest.cell("glm-4.7-flash-decode.serve-reason-closed", root=root)
    names = [m["name"] for m in cell.per_layer]
    # what the other serving cells report and this one has nothing to read for
    absent = {"kernel.mla_decode_roofline", "serve.latent_pool_used_pct",
              "model.decode_ssm_device_pct", "kernel.ssm_decode_roofline",
              "serve.recurrent_state_gb"}
    assert not absent & set(names)
    # what every serving cell reports, the expert metrics of the MoE
    # serving cells, the mean context, and four of its own
    shared = _tiny.EVERY_SERVING_CELL + ["serve.mean_context_tokens"]
    experts = _tiny.EXPERT_SERVING_CELLS
    assert set(names) >= set(shared) | set(experts) | set(OWN)
    assert set(shared) <= {m["name"] for m in jamba.per_layer}
    assert in_order(OWN, names)
    assert set(experts) <= {m["name"] for m in glm.per_layer}
    assert [m["name"] for m in cell.end_to_end] == [
        "serve_tokens_per_s", "serve_ttft_p95_ms", "serve_tpot_p95_ms",
        "setup_s",
    ]
    assert cell.chips == 1
    assert cell.config["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert cell.config["share"] == {
        "published": {"n_routed_experts": 256, "vocab_size": 152_576}}
    assert cell.config["serving"] == {
        "slots": 256, "page_size": 64, "decode_max_length": 1152,
    }
    # the ring wraps at the tiny size: window 6, pages of 4, contexts to 26
    assert cell.config["tiny"]["serving"] == {
        "slots": 4, "page_size": 4, "decode_max_length": 32,
    }
    assert cell.traffic_name == "serve-reason-closed"
    # the same table of requests as the other two reasoning cells
    assert cell.traffic == jamba.traffic == glm.traffic


def test_the_manifest_gives_the_cell_its_metrics():
    check_the_manifest_gives_the_cell_its_metrics()


OTHERS_AT_PR_43 = [
    "qwen3-30b-a3b-l1.train-16k", "qwen3-30b-a3b-decode.serve-rollout-closed",
    "deepseek-v2-lite-l2.train-16k", "qwen3-30b-a3b-ep4.train-16k",
    "glm-4.7-flash-decode.serve-reason-closed", JAMBA,
    "xing4.0-29b-a4b-share8.train-8k",
]
OWN_FILES = [
    ("serve.window_cache_gb", "GB", "program_counter", "serving loop",
     "serve_tokens_per_s", "lower"),
    ("model.decode_window_attention_device_pct", "%", "device_trace",
     "model", "serve_tpot_p95_ms", "lower"),
    ("kernel.gqa_decode_roofline", "%", "device_trace", "kernels",
     "serve_tokens_per_s", "higher"),
    ("moe.decode_held_rows_pct", "%", "program_counter", "model",
     "serve_tokens_per_s", "higher"),
]


def check_an_own_metrics_file_is_listed_for_its_cell(
        name, unit, source, layer, moves, better, root=ROOT):
    """Listed since PR 43 for this cell alone, entries alone: a file
    holds what its entry repeats, and a reader
    (``test_gqa_decode_cost.py`` has each over a hand-made run)."""
    from benchmarks.harness import manifest

    assert name in OWN
    by_name = {m["name"]: m for m in manifest.manifest(root)["per_layer"]}
    entry = by_name[name]
    # for this cell and for none of the other cells of PR 43: none of them
    # decodes through a window layer or a held range of experts (a later
    # cell that does may join)
    assert CELL in entry["workloads"]
    assert not set(OTHERS_AT_PR_43) & set(entry["workloads"])
    own = manifest.metric_file(name, root=root)
    assert own["name"] == name and own["reader"] == {"file": True}
    assert (root / "benchmarks/metrics" / f"{name}.py").is_file()
    for key in ("unit", "better", "source", "layer", "moves"):
        assert entry[key] == own[key], key
    assert (own["unit"], own["source"], own["layer"], own["moves"],
            own["better"]) == (unit, source, layer, moves, better)
    assert not {"kinds", "min_chips", "workloads"} & set(own)


@pytest.mark.parametrize("name,unit,source,layer,moves,better", OWN_FILES)
def test_an_own_metrics_file_is_listed_for_its_cell(
        name, unit, source, layer, moves, better):
    check_an_own_metrics_file_is_listed_for_its_cell(
        name, unit, source, layer, moves, better)
